"""DFOGraph engine: vertex-centric push with signal/slot (paper §3).

ProcessEdges runs the paper's four phases:
  1. generating          — active vertices produce messages (``signal``),
  2. inter-node pass     — messages are *filtered* (paper §4.3) and exchanged
                           between partitions,
  3. intra-node dispatch — messages are routed to destination batches using
                           the dispatching graph (= the DCSR arrays, §4.2),
  4. processing          — ``slot`` contributions along edges are combined per
                           destination vertex and ``apply`` updates vertex state.

The phase implementations live in :mod:`repro.core.phases`; the four
executors that compose them live in :mod:`repro.core.executor`:
  * ``LOCAL``     — one device; the partition axis is a leading array axis;
    "network" traffic is accounted by counters (what *would* cross the wire).
  * ``SHARD_MAP`` — the partition axis is a mesh axis; the inter-node pass is
    a real ``lax.all_to_all`` on the interconnect.
  * ``OOC``       — single host, disk-resident chunks + vertex spill with
    measured I/O cross-checked against the model (DESIGN.md §6).
  * ``DIST_OOC``  — W workers with their own chunk-store shards and spills;
    the inter-node pass is a need-list-filtered sparse exchange with
    adaptively encoded, *measured* wire bytes (DESIGN.md §7).
They differ only in how the exchange is realized and counters are reduced.

TPU adaptation of the slot guarantee: the C++ system serializes slot calls
per destination vertex (so no atomics are needed).  Here ``slot``
contributions are reduced with a user-chosen **associative + commutative
monoid** (add/min/max — all four paper algorithms fit), the data-race-free
equivalent on a parallel machine.  See DESIGN.md §2.

Phase 4 runs on a configurable compute backend
(``EngineConfig.compute_backend``): the flat ``"segment"`` reference, or
``"block_csr"`` — the Pallas block-CSR kernel over per-(source partition,
destination batch) tiles that zero-skips chunks which received no messages
(selective computation, §4.1/§4.4, realized on the compute path).

Counters use float32: per-iteration magnitudes in our experiments stay far
below 2**24; benchmark drivers accumulate across iterations in Python floats.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from collections.abc import Mapping
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import os

from repro.core import executor as _executor
from repro.core import multiquery as _multiquery
from repro.core.chunkstore import (
    ChunkStore, DiskChunkSource, HBMChunkSource, ShardedChunkStore,
    VertexSpill, device_decode_fits,
)
from repro.core.exchange import WIRE_MSG_BYTES
from repro.core.formats import ChunkFormats, build_block_tiles
from repro.core.partition import DistGraph
from repro.core.phases import (
    batch_touched, bitmap_model_bytes, reduce_worker_counters,
)
from repro.core.tracing import span
from repro.utils import pack_bools, token_ctx, unpack_bools

State = Dict[str, jnp.ndarray]      # name -> [P, V] stacked vertex arrays


# ---------------------------------------------------------------------------
# Monoids
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Monoid:
    name: str
    identity: float

    def segment(self, data, segment_ids, num_segments):
        if self.name == "add":
            return jax.ops.segment_sum(data, segment_ids, num_segments)
        if self.name == "min":
            return jax.ops.segment_min(data, segment_ids, num_segments)
        if self.name == "max":
            return jax.ops.segment_max(data, segment_ids, num_segments)
        raise ValueError(self.name)


ADD = Monoid("add", 0.0)
MIN = Monoid("min", float(np.finfo(np.float32).max))
MAX = Monoid("max", float(np.finfo(np.float32).min))


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Tunables mirroring the paper's knobs (plus this repo's executor and
    audit switches).  See README.md for the executor matrix and DESIGN.md
    §6–§8 for the out-of-core, distributed, and parallel-pipeline layers."""

    enable_filtering: bool = True
    """Apply the paper's §4.3 need-list message filter in phase 2: a
    message travels to destination partition q only if q actually has an
    in-edge from its source vertex.  Off = every active message is sent to
    every partition (the Chaos-like behavior the paper improves on)."""

    filter_skip_threshold: float = 2.0
    """Skip the filter toward a destination when its need list is not
    substantially smaller than the message file — send everything once
    ``|L_pq| >= threshold * |M_p]``.  2.0 is the paper's heuristic: below
    a 2x reduction the filter costs more than it saves."""

    msg_bytes: int = 4
    """Payload bytes per message value in the I/O and network byte models.
    The dist_ooc executor serializes float32 values on a real wire, so it
    requires the wire's 4 (validated at Engine construction)."""

    enable_adaptive_formats: bool = True
    """Per-chunk runtime CSR/DCSR selection (paper §4.1): each active chunk
    is read in whichever representation the seek-cost model prices cheaper
    for this iteration's message density.  Required by the ooc / dist_ooc
    executors — their physical reads follow the same decision, which is
    what makes measured bytes equal the model."""

    account_io: bool = True
    """Maintain the modeled I/O counters (vertex/edge/bitmap bytes).
    Required by the ooc / dist_ooc executors: the measured-vs-modeled
    cross-check needs both sides."""

    compression: bool = True
    """The §4.1 compression tier (DESIGN.md §9), applied to storage *and*
    wire: per-chunk reads arbitrate a three-way {CSR-pruned, DCSR-raw,
    DCSR-delta} choice over the compressed columnar layout (dst column
    pruned to its delta-varint residues, DCSR pairs optionally delta-varint
    encoded), and cross-worker message batches add a delta-varint pair
    encoding to the pairs/slab wire choice.  ``edge_read_bytes`` /
    ``net_bytes`` then price the compressed sizes; their ``*_raw`` twins
    keep the uncompressed pricing for the Fig.5-style ratio.  The ooc /
    dist_ooc executors require a store built with the same flag
    (``ChunkStore.build(..., compression=...)``, validated).  Algorithm
    results are bit-identical with the knob on or off — only bytes
    (modeled and measured alike) change."""

    compute_backend: str = "segment"
    """Phase-4 combine implementation: ``"segment"`` (flat per-edge gather
    + segment reduction; the reference) or ``"block_csr"`` (the Pallas
    block-CSR tile kernel with zero-skipping of chunks that received no
    messages — DESIGN.md §4).  Non-affine slot functions fall back to
    segment with a warning.  Note: the ooc / dist_ooc executors evaluate
    ``slot_fn`` on host **numpy** arrays per streamed batch, for either
    backend (those calls must not route through jax's eager dispatch,
    which serializes parallel workers — DESIGN.md §8 — and compiles per
    batch length); write slots as plain
    array arithmetic, valid for numpy and jnp operands alike, as all four
    paper algorithms do."""

    block_tile: int = 8
    """Tile edge length T for the block_csr backend (tiles are [T, T])."""

    executor: str = "auto"
    """Which executor realizes ProcessEdges: ``"auto"`` picks LOCAL (no
    mesh) or SHARD_MAP (a mesh was passed); ``"ooc"`` streams disk-resident
    chunks on one host (requires ``store=ChunkStore.build(...)``);
    ``"dist_ooc"`` runs W workers over per-worker chunk shards (requires
    ``store=ChunkStore.build_sharded(...)`` and ``num_workers``)."""

    verify_io: bool = True
    """For ooc / dist_ooc: raise inside every call if any measured counter
    (disk bytes, chunks, and — dist_ooc — network bytes) deviates from the
    analytic model.  The repo's signature invariant; leave it on."""

    ooc_prefetch_depth: int = 2
    """How many decoded dst-batch work items the chunk prefetch thread may
    run ahead of the combine (2 = classic double buffering)."""

    num_workers: int = 1
    """W for ``executor="dist_ooc"``: each worker owns a contiguous block
    of P / W destination partitions (P % W == 0, validated) backed by its
    own chunk-store shard and vertex spill."""

    parallel_workers: bool = False
    """dist_ooc only (validated): run the W per-worker send loops and
    receive pipelines on per-phase thread pools so workers overlap each
    other's disk reads, exchange decode, and combine (DESIGN.md §8).
    Results are bit-identical to sequential execution — counters are
    reduced in worker index order after each phase joins — so this is
    purely a wall-clock knob; ``benchmarks/table7_scaling.py`` reports the
    sequential-vs-parallel times side by side."""

    device_decode: bool | None = None
    """ooc / dist_ooc, compressed stores only: decode chunk payloads with
    the Pallas varint/delta kernels (``kernels/varint.py``) instead of the
    host numpy codec (DESIGN.md §10).  The decode becomes a chain of jit
    dispatches that release the GIL, so prefetch threads skip the compute
    token for it; bytes read from disk, the byte model, and the decoded
    triples are bit-identical either way — only where the byte-unpacking
    runs changes.  ``None`` (auto) enables it exactly when the Pallas
    kernels would compile rather than run interpreted (i.e. a real
    accelerator backend is present, same auto-selection as
    ``kernels/csr_spmv.py``); uncompressed stores always decode on the
    host (their payload is a plain memcpy, nothing to decode), and so do
    stores whose partitions overflow the kernels' int32 domain
    (``chunkstore.device_decode_fits``; ``True`` raises for them)."""

    physical_sparse_exchange: bool | None = None
    """SHARD_MAP only: realize the adaptive wire physically (DESIGN.md
    §12).  The executor fixes a short ladder of power-of-two per-peer
    capacities when it is built (``executor.wire_ladder``: the largest
    capacity at which ``exchange.choose_physical_exchange`` still prefers
    the compacted collective, then down by a constant factor), and its one
    compiled step picks, every iteration, the first rung that holds the
    ``pmax``'d max per-(p, q) live count of the send decision, else the
    dense slab.  A compacted rung ships ``capacity`` (value,
    source-index) pairs per peer (the multi-query panel adds per-query
    presence flags over ONE shared index stream) and re-densifies on
    receipt, so results are bit-identical to the dense exchange; the
    shipped payload is reported as the ``net_payload_elems`` /
    ``measured_net_payload_elems`` counter pair and cross-checked under
    ``verify_io``.  ``None`` (auto) enables it exactly when a mesh is
    passed; ``False`` keeps the dense slab (the tests' reference);
    ``True`` without a mesh is an error (the other executors have no
    in-mesh collective to realize)."""

    num_queries: int = 1
    """Q for the multi-query serving surface (``process_edges_multi`` /
    ``process_vertices_multi``, DESIGN.md §11): vertex state carries a
    trailing query axis ([P, V, Q] panels) and ONE selective pass serves
    all Q frontiers — the scheduled active set is the union of the
    per-query frontiers, per-query masks keep the combines independent.
    The ooc / dist_ooc vertex spills are laid out per query
    (``{key}@q{j}`` columns, ``active_q{j}`` bitmaps), so a spill root
    must be (re)built with the same Q (``VertexSpill`` validates).  The
    single-query API is unaffected by this knob."""


COUNTER_KEYS = (
    "msgs_generated", "msgs_sent", "msgs_sent_nofilter",
    "net_bytes", "net_bytes_raw", "net_bytes_nofilter",
    "msgs_dispatched", "edges_touched", "chunks_read",
    "chunks_read_csr", "chunks_read_dcsr", "chunks_read_dcsr_delta",
    "edge_read_bytes", "edge_read_bytes_raw",
    "vertex_read_bytes", "vertex_write_bytes",
    "msg_disk_bytes", "seek_cost",
    # SHARD_MAP physical wire (DESIGN.md §12; zero on the executors whose
    # exchange is not an in-mesh collective): payload ELEMENTS the chosen
    # collective moves (model), its measured twin derived from the shipped
    # array shapes, the dense-slab reference volume of the same
    # iterations, and how many iterations each physical path carried.
    "net_payload_elems", "net_payload_elems_dense",
    "measured_net_payload_elems",
    "exchange_compacted_iters", "exchange_dense_iters",
)

# Measured twins of the modeled I/O counters, reported by the OOC/dist_ooc
# executors (what the storage tier actually served) and cross-checked
# against the analytic model when EngineConfig.verify_io is on.
MEASURED_KEYS = (
    "measured_chunks_read", "measured_edge_read_bytes",
    "measured_vertex_read_bytes", "measured_vertex_write_bytes",
    # how many of the measured chunk reads were decoded by the Pallas
    # kernels (EngineConfig.device_decode), and in how many decode calls
    # (one per dst batch read); no analytic twin — they report the decode
    # path taken, not bytes moved
    "measured_chunks_device_decoded", "measured_device_decode_calls",
)

MEASURED_PAIRS = (
    ("measured_chunks_read", "chunks_read"),
    ("measured_edge_read_bytes", "edge_read_bytes"),
    ("measured_vertex_read_bytes", "vertex_read_bytes"),
    ("measured_vertex_write_bytes", "vertex_write_bytes"),
)

# dist_ooc additionally audits the wire: bytes physically serialized across
# workers vs the analytic network model, plus which adaptive encoding each
# cross-worker message batch chose.
DIST_MEASURED_KEYS = (
    "measured_net_bytes", "net_pair_batches", "net_vpair_batches",
    "net_slab_batches", "net_uval_batches",
)

DIST_MEASURED_PAIRS = MEASURED_PAIRS + (
    ("measured_net_bytes", "net_bytes"),
)

# The SHARD_MAP executor's wire audit (DESIGN.md §12): the physical
# collective's payload-element volume must equal the model that arbitrated
# it, checked after every distributed ProcessEdges when verify_io is on.
SHARDED_MEASURED_PAIRS = (
    ("measured_net_payload_elems", "net_payload_elems"),
)


class _BlockState(Mapping):
    """Mapping view of per-worker spill blocks as one [P, V] state.

    Each value concatenates the workers' contiguous partition rows on
    first access (cached thereafter).  Like the OOC executor's memmap
    views, the underlying storage is authoritative: values reflect the
    spills as of first access, and states are consumed before the next
    engine call mutates them (the algorithms' usage pattern)."""

    def __init__(self, views: list):
        self._views = views
        self._cache: dict = {}

    def __getitem__(self, key):
        if key not in self._cache:
            self._cache[key] = np.concatenate(
                [v[key] for v in self._views], axis=0)
        return self._cache[key]

    def __iter__(self):
        return iter(self._views[0])

    def __len__(self):
        return len(self._views[0])


def zero_counters() -> Dict[str, jnp.ndarray]:
    return {k: jnp.zeros((), jnp.float32) for k in COUNTER_KEYS}


def accumulate_counters(acc: dict, new: dict) -> dict:
    """Host-side accumulation across iterations (python floats)."""
    return {k: acc.get(k, 0.0) + float(new[k]) for k in new}


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class Engine:
    """Executes signal/slot programs over a two-level-partitioned graph."""

    counter_keys = COUNTER_KEYS

    def __init__(self, graph: DistGraph, fmts: ChunkFormats,
                 config: EngineConfig = EngineConfig(),
                 mesh: Mesh | None = None, axis: str = "part",
                 store: ChunkStore | None = None,
                 proc_ctx=None):
        self.graph = graph
        self.fmts = fmts
        self.config = config
        self.mesh = mesh
        self.axis = axis
        self.proc_ctx = proc_ctx
        if proc_ctx is not None and config.executor != "dist_ooc":
            raise ValueError(
                "proc_ctx (multi-process transport, DESIGN.md §13) applies "
                f"only to executor='dist_ooc', got {config.executor!r}")
        spec = graph.spec
        bounds = np.asarray(spec.boundaries)
        gid = np.zeros((spec.num_partitions, spec.v_max), np.int32)
        for p in range(spec.num_partitions):
            gid[p] = bounds[p] + np.arange(spec.v_max)
        self.global_id = jnp.asarray(gid)           # [P, V]
        self._distributed = mesh is not None
        self.source = HBMChunkSource(graph, fmts)
        self.counter_keys = COUNTER_KEYS
        if config.executor == "ooc":
            self.counter_keys = COUNTER_KEYS + MEASURED_KEYS
        elif config.executor == "dist_ooc":
            self.counter_keys = (COUNTER_KEYS + MEASURED_KEYS
                                 + DIST_MEASURED_KEYS)
        # OOC / dist_ooc executor state (DESIGN.md §6, §7)
        if config.executor not in ("auto", "ooc", "dist_ooc"):
            raise ValueError(f"unknown executor: {config.executor!r}")
        if config.num_queries < 1:
            raise ValueError(
                f"num_queries must be >= 1, got {config.num_queries}")
        if config.parallel_workers and config.executor != "dist_ooc":
            raise ValueError(
                "parallel_workers applies only to executor='dist_ooc' (the "
                "other executors have no per-worker loops to overlap); got "
                f"executor={config.executor!r}")
        self._ooc = config.executor == "ooc"
        self._dist_ooc = config.executor == "dist_ooc"
        self._measured_pairs = (DIST_MEASURED_PAIRS if self._dist_ooc
                                else MEASURED_PAIRS)
        self.store = store
        # Resolve the device_decode knob (docstring on EngineConfig): auto
        # means "on exactly when the Pallas kernels would compile", and the
        # flag is only meaningful on the executors that decode compressed
        # chunk payloads.
        if config.device_decode and not config.compression:
            raise ValueError(
                "device_decode=True requires compression=True: uncompressed "
                "chunk payloads are plain column memcpys with nothing to "
                "decode on device")
        fits = device_decode_fits(spec.partition_sizes())
        if config.device_decode and (self._ooc or self._dist_ooc) \
                and not fits:
            raise ValueError(
                "device_decode=True needs num_partitions x the largest "
                "partition's size, rounded up to a power of two, below "
                "2**31 (the kernels' int32 domain); use more, smaller "
                "partitions or the host decode")
        if config.device_decode is None:
            from repro.kernels.csr_spmv import default_interpret
            self.device_decode = (config.compression
                                  and (self._ooc or self._dist_ooc)
                                  and fits and not default_interpret())
        else:
            self.device_decode = bool(config.device_decode)
        # Resolve the physical_sparse_exchange knob (docstring on
        # EngineConfig): auto means "on exactly when there is a mesh for
        # the collective to run over".
        if config.physical_sparse_exchange and not self._distributed:
            raise ValueError(
                "physical_sparse_exchange=True requires the SHARD_MAP "
                "executor (pass mesh=...): the other executors have no "
                "in-mesh collective to realize")
        if config.physical_sparse_exchange is None:
            self.physical_sparse_exchange = self._distributed
        else:
            self.physical_sparse_exchange = bool(
                config.physical_sparse_exchange)
        if self._ooc or self._dist_ooc:
            name = config.executor
            if self._distributed:
                raise ValueError(f"executor={name!r} is single-process; "
                                 "the SHARD_MAP executor is selected by "
                                 "`mesh`")
            if not config.enable_adaptive_formats:
                raise ValueError(
                    f"executor={name!r} requires enable_adaptive_formats: "
                    "the non-adaptive model prices DCSR-only chunks at 0 "
                    "bytes, which no physical read can match")
            if not config.account_io:
                raise ValueError(f"executor={name!r} requires account_io "
                                 "(the measured/modeled cross-check needs "
                                 "both)")
            self._ooc_last_state = None
            self._mq_last_state = None

        def check_store_spec(manifest, root):
            """A store built for a different partitioning must fail here
            with a clear error, not via oblique slicing downstream."""
            got = tuple(manifest.get(k) for k in
                        ("num_partitions", "num_batches", "batch_size",
                         "v_max"))
            want = (spec.num_partitions, spec.num_batches,
                    spec.batch_size, spec.v_max)
            if got != want:
                raise ValueError(
                    f"chunk store at {root} was built for a different "
                    f"partitioning (P, B, batch_size, v_max) = {got}; "
                    f"this graph's spec has {want}")
            stored = bool(manifest.get("compression", False))
            if stored != config.compression:
                raise ValueError(
                    f"chunk store at {root} was built with "
                    f"compression={stored}, but EngineConfig.compression="
                    f"{config.compression}; the physical layout must match "
                    "the byte model (rebuild the store or flip the knob)")
            elided = bool(manifest.get("values_elided", False))
            want_elided = config.compression and bool(
                getattr(fmts, "values_elided", False))
            if elided != want_elided:
                raise ValueError(
                    f"chunk store at {root} has values_elided={elided}, but "
                    f"this graph's formats price values_elided={want_elided}"
                    "; the physical layout must match the byte model "
                    "(rebuild the store from these formats)")

        if self._ooc:
            if not isinstance(store, ChunkStore):
                raise ValueError("executor='ooc' requires a ChunkStore "
                                 "(ChunkStore.build(graph, fmts, root))")
            check_store_spec(store.manifest, store.root)
            self.ooc_source = DiskChunkSource(store, graph, fmts)
            self.spill = VertexSpill(
                os.path.join(store.root, "vertex"), spec.num_partitions,
                spec.num_batches, spec.batch_size, spec.v_max,
                num_queries=config.num_queries)
        if self._dist_ooc:
            if not isinstance(store, ShardedChunkStore):
                raise ValueError(
                    "executor='dist_ooc' requires a ShardedChunkStore "
                    "(ChunkStore.build_sharded(graph, fmts, root, W))")
            if store.num_workers != config.num_workers:
                raise ValueError(
                    f"num_workers={config.num_workers} does not match the "
                    f"sharded store's {store.num_workers} worker shards")
            if config.msg_bytes != WIRE_MSG_BYTES:
                raise ValueError(
                    f"executor='dist_ooc' serializes float32 message values "
                    f"on the wire; msg_bytes must be {WIRE_MSG_BYTES} so "
                    "measured network bytes can equal the model")
            for s in store.shards:
                check_store_spec(s.manifest, s.root)
            self.worker_parts = [tuple(s.partitions) for s in store.shards]
            self.worker_of = store.worker_of
            self.dist_sources = [DiskChunkSource(s, graph, fmts)
                                 for s in store.shards]
            self.spills = [VertexSpill(
                os.path.join(s.root, "vertex"), len(parts),
                spec.num_batches, spec.batch_size, spec.v_max,
                num_queries=config.num_queries)
                for s, parts in zip(store.shards, self.worker_parts)]
            self.reset_worker_totals()
            if proc_ctx is not None:
                # Process-mode dist_ooc: this engine replica executes only
                # the logical workers proc_ctx assigns to this rank; the
                # transport carries cross-rank batches, and recoverable()
                # wraps every op with a per-op blockstore checkpoint so a
                # peer's crash rolls the op back bit-identically
                # (DESIGN.md §13).
                if proc_ctx.num_workers != config.num_workers:
                    raise ValueError(
                        f"proc_ctx has num_workers={proc_ctx.num_workers} "
                        f"but EngineConfig.num_workers={config.num_workers}")
                if config.num_queries != 1:
                    raise ValueError(
                        "process-mode dist_ooc supports num_queries=1 only "
                        "(the recovery checkpoint covers the single-query "
                        "spill layout)")
                self._ckpt_stores = {}
                self._proc_wt_snap = None
                proc_ctx.register_engine(self)
            # Long-lived phase pool (parallel_workers): one thread per
            # worker, reused by every ProcessEdges / ProcessVertices phase
            # barrier; idle threads exit when the engine is collected.
            self.worker_pool = (
                ThreadPoolExecutor(max_workers=config.num_workers,
                                   thread_name_prefix="dist-worker")
                if config.parallel_workers else None)
            # Second long-lived pool hosting the per-worker pipeline loops
            # (one prefetcher + one decode task per worker, DESIGN.md §8)
            # so parallel iterations reuse warm threads instead of
            # spawning 2 * W fresh ones each.
            self.pipeline_pool = (
                ThreadPoolExecutor(max_workers=2 * config.num_workers,
                                   thread_name_prefix="dist-pipeline")
                if config.parallel_workers else None)
        # block_csr backend state (built lazily on first use)
        self._block = None
        self._block_host = None
        self._block_garrs = None
        self._block_vals_cache: dict = {}
        self._probe_cache: dict = {}
        self._pe_cache: dict = {}
        self._pv_cache: dict = {}
        self._warned_slot_fallback = False
        if self._distributed:
            self._shard = NamedSharding(mesh, P(axis))
            put = lambda x: jax.device_put(x, self._shard)
            self._garrs = dict(
                vertex_valid=put(graph.vertex_valid),
                need=put(graph.need),
                need_counts=put(graph.need_counts),
                global_id=put(self.global_id),
                **{k: put(v) for k, v in
                   HBMChunkSource.dest_arrays(fmts).items()},
                **{k: put(v) for k, v in
                   HBMChunkSource.edge_arrays(graph).items()},
            )

    def init_state(self, **arrays: jnp.ndarray) -> State:
        state = {k: jnp.asarray(v) for k, v in arrays.items()}
        if self._distributed:
            state = {k: jax.device_put(v, self._shard) for k, v in state.items()}
        return state

    # -- OOC / dist_ooc state residency -------------------------------------
    def _sync_ooc_state(self, state: State) -> None:
        """Make the spill(s) authoritative for ``state``.

        States returned by OOC/dist calls are recognized by identity and
        skipped (the spills already hold them); anything else — the initial
        ``init_state`` dict or caller-constructed arrays — is loaded as an
        unmeasured preprocessing sync."""
        if state is self._ooc_last_state:
            return
        self._mq_last_state = None
        arrs = {k: np.asarray(v) for k, v in state.items()}
        valid = np.asarray(self.graph.vertex_valid)
        if self._dist_ooc:
            # Process mode: this rank materializes only its owned workers'
            # spills (the others live on their owning ranks' disks).
            workers = (self.proc_ctx.my_workers() if self.proc_ctx is not None
                       else range(len(self.worker_parts)))
            for w in workers:
                parts = self.worker_parts[w]
                lo, hi = parts[0], parts[-1] + 1
                self.spills[w].load({k: v[lo:hi] for k, v in arrs.items()})
                self.spills[w].write_bitmap(valid[lo:hi])
                self.spills[w].reset_io_counters()
            return
        self.spill.load(arrs)
        self.spill.write_bitmap(valid)
        self.spill.reset_io_counters()

    def _sync_mq_state(self, state) -> None:
        """Multi-query twin of :meth:`_sync_ooc_state`: make the spill(s)
        authoritative for a [P, V, Q] state panel, flattened to the
        per-query ``{key}@q{j}`` columns with one ``active_q{j}`` bitmap
        each.  Panels returned by multi-query OOC/dist calls are
        recognized by identity and skipped; anything else loads as an
        unmeasured preprocessing sync."""
        if state is self._mq_last_state:
            return
        self._ooc_last_state = None
        nq = self.config.num_queries
        arrs = {k: np.asarray(v) for k, v in state.items()}
        valid = np.asarray(self.graph.vertex_valid)

        def load_one(spill, lo, hi):
            spill.load({f"{k}@q{j}": v[lo:hi, :, j]
                        for k, v in arrs.items() for j in range(nq)})
            for j in range(nq):
                spill.write_bitmap(valid[lo:hi], name=f"active_q{j}")
            spill.reset_io_counters()

        if self._dist_ooc:
            for w, parts in enumerate(self.worker_parts):
                load_one(self.spills[w], parts[0], parts[-1] + 1)
            return
        load_one(self.spill, 0, self.graph.spec.num_partitions)

    def _dist_state_views(self) -> State:
        """Lazy [P, V] state over the per-worker spills (the worker blocks
        are contiguous partition ranges, in order).  Intermediate
        iterations only identity-check the returned state, so the
        per-key concatenation is deferred to first access — like the OOC
        executor's zero-copy views, the full vertex state is never
        materialized unless a caller actually reads it.

        Process mode returns a padded plain dict instead: only this rank's
        owned rows are filled (the rest are zeros, never read — drivers
        identity-pass the state back in and the final values are assembled
        by gathering owned slices across ranks)."""
        if self.proc_ctx is not None:
            spec = self.graph.spec
            mine = self.proc_ctx.my_workers()
            out: dict = {}
            first = self.spills[mine[0]].state_views()
            for name, arr0 in first.items():
                out[name] = np.zeros((spec.num_partitions, spec.v_max),
                                     arr0.dtype)
            for w in mine:
                parts = self.worker_parts[w]
                lo, hi = parts[0], parts[-1] + 1
                for name, arr in self.spills[w].state_views().items():
                    out[name][lo:hi] = arr
            return out
        return _BlockState([sp.state_views() for sp in self.spills])

    # -- process-mode recovery hooks (DESIGN.md §13) -------------------------
    def _proc_ckpt_store(self, w: int):
        """Per-worker BlockStore under the worker's shard root (shared
        disk), so an adopting rank reads the checkpoints the dead rank
        wrote.  Keyed by the run id: concurrent runs over one store root
        never mix manifests."""
        store = self._ckpt_stores.get(w)
        if store is None:
            from repro.ckpt.blockstore import BlockStore
            root = os.path.join(self.store.shards[w].root,
                                f"ckpt-{self.proc_ctx.run_id}")
            store = self._ckpt_stores[w] = BlockStore(root, keep=2)
        return store

    def _proc_ckpt_save(self, op: int) -> None:
        """Checkpoint this rank's owned spills at the start of op ``op``
        (called by ``ProcContext.recoverable`` *before* the ready
        barrier, so every injected kill point — all post-barrier — leaves
        ckpt(op) on shared disk for the adopter).  Content-addressed
        blocks make the unchanged arrays free (paper §3.2).  Also
        snapshots ``worker_totals`` in memory: a failed attempt's partial
        per-worker accumulation must not leak into the replay."""
        ctx = self.proc_ctx
        self._proc_wt_snap = [dict(d) for d in self.worker_totals]
        for w in ctx.my_workers():
            spill = self.spills[w]
            tree = {"s:" + name: np.array(arr)
                    for name, arr in spill.state_views().items()}
            bm = spill.read_bitmap(measured=False)
            if bm is not None:
                tree["active"] = bm
            self._proc_ckpt_store(w).save(tree, step=op)

    def _proc_rollback(self, op: int) -> None:
        """Restore every owned spill (and ``worker_totals``) to the
        pre-op checkpoint so the op can replay bit-identically on the
        re-planned ownership.  Restores are unmeasured: the replay
        re-issues the exact measured I/O the failure-free run would
        have."""
        ctx = self.proc_ctx
        if self._proc_wt_snap is not None:
            self.worker_totals = [dict(d) for d in self._proc_wt_snap]
        for w in ctx.my_workers():
            spill = self.spills[w]
            store = self._proc_ckpt_store(w)
            if op in store.steps():
                tree = store.restore(op)
                spill.load({k[len("s:"):]: v for k, v in tree.items()
                            if k.startswith("s:")})
                if "active" in tree:
                    spill.write_bitmap(tree["active"].astype(bool),
                                       measured=False)
                else:
                    bits = os.path.join(spill.root, "active.bits")
                    if os.path.exists(bits):
                        os.remove(bits)
            else:
                # Defensive: an adopted worker whose owner died before
                # saving ckpt(op) — impossible for the injected kill
                # points (all post-barrier) — attaches the on-disk state
                # as the dead rank last left it.
                spill.attach()

    def _proc_resume_restore(self, resume_op: int) -> None:
        """Whole-job resume: put this rank's owned spills in the exact
        post-``resume_op`` state (called by ``ProcContext.prepare_resume``
        before any op replays).

        Per worker, in preference order: the checkpoint saved at the
        start of op ``resume_op + 1`` (its pre-op content IS the
        post-``resume_op`` state — this engine ran the op the crash
        interrupted, so its spill files may hold that op's partial
        mutations); defensively, the latest checkpoint of any other
        never-committed op (> ``resume_op``); else the on-disk spill
        files exactly as the crashed incarnation last committed them
        (engines untouched since their last committed op).  A checkpoint
        of a *committed* op is never restored — it would roll that op
        back.  Engines whose spills were never materialized (crash before
        their first op) have nothing to restore: the live replay's first
        ``_sync_ooc_state`` loads the driver's initial state as usual."""
        ctx = self.proc_ctx
        for w in ctx.my_workers():
            spill = self.spills[w]
            store = self._proc_ckpt_store(w)
            steps = store.steps()
            target = None
            if resume_op + 1 in steps:
                target = resume_op + 1
            elif steps and max(steps) > resume_op:
                target = max(steps)
            if target is not None:
                tree = store.restore(target)
                spill.load({k[len("s:"):]: v for k, v in tree.items()
                            if k.startswith("s:")})
                if "active" in tree:
                    spill.write_bitmap(tree["active"].astype(bool),
                                       measured=False)
                else:
                    bits = os.path.join(spill.root, "active.bits")
                    if os.path.exists(bits):
                        os.remove(bits)
            elif spill.on_disk():
                spill.attach()
            else:
                continue
            spill.reset_io_counters()

    def _proc_adopt_workers(self, adopted, in_op: bool) -> None:
        """Take over the listed logical workers after recovery re-planned
        them onto this rank: re-open their chunk shards (immutable files,
        fresh manifest validation) and rebuild the per-worker disk
        sources.  For the engine whose op is being recovered, the spill
        itself is restored by the subsequent ``_proc_rollback``; for any
        other registered engine (wcc runs two over one context) the dead
        rank's spill files are consistent as of that engine's last
        committed op, so attaching them in place is exact."""
        for w in adopted:
            self.store.reopen_shard(w)
            self.dist_sources[w] = DiskChunkSource(
                self.store.shards[w], self.graph, self.fmts)
            if not in_op:
                self.spills[w].attach()

    def reset_worker_totals(self) -> None:
        """Per-worker measured traffic accumulated across calls (the
        max-per-worker quantities of the scaling benchmark), plus
        ``worker_times`` — per-worker wall clock spent in each phase
        (send / receive pipelines of ProcessEdges, ProcessVertices).
        Timings live beside, not inside, ``worker_totals`` so the
        traffic totals stay bit-identical between sequential and
        parallel runs."""
        self.worker_totals = [
            dict(disk_bytes=0.0, net_bytes=0.0, edges_touched=0.0)
            for _ in range(self.config.num_workers)]
        self.worker_times = [
            dict(send_s=0.0, recv_s=0.0, pv_s=0.0)
            for _ in range(self.config.num_workers)]

    def _check_measured(self, counters: dict, pairs=None) -> None:
        """Cross-check measured storage (and, for dist_ooc, network)
        traffic against the analytic model (the fully-out-of-core claim,
        enforced every call).  ``pairs`` overrides the executor's default
        pair set — the SHARD_MAP paths pass ``SHARDED_MEASURED_PAIRS`` to
        audit the physical collective's payload-element volume."""
        if not self.config.verify_io:
            return
        for mk, ak in (self._measured_pairs if pairs is None else pairs):
            if abs(float(counters[mk]) - float(counters[ak])) > 0.5:
                raise RuntimeError(
                    f"{self.config.executor} measured/model I/O mismatch: "
                    f"{mk}={counters[mk]:.1f} vs {ak}={counters[ak]:.1f}")

    # -- block_csr backend plumbing ----------------------------------------
    def _ensure_block(self):
        if self._block is None:
            self._block, self._block_host = build_block_tiles(
                self.graph, tile=self.config.block_tile)
            if self._distributed:
                self._block_garrs = jax.device_put(self._block, self._shard)

    def _probe_slot(self, slot_fn, monoid):
        """Cached affine-slot probe; warns once and returns None when the
        slot cannot be lowered to tiles (segment fallback)."""
        pkey = _executor.slot_probe_key(slot_fn, monoid)
        if pkey is not None and pkey in self._probe_cache:
            probe = self._probe_cache[pkey]
        else:
            probe = _executor.probe_slot_affine(
                slot_fn, monoid, np.asarray(self.graph.edge_data),
                np.asarray(self.graph.edge_valid))
            if pkey is not None:
                self._probe_cache[pkey] = probe
        if probe is None and not self._warned_slot_fallback:
            warnings.warn(
                "compute_backend='block_csr' requires slot(m, d) affine "
                "in m (constant slope for min/max); falling back to the "
                "segment backend for this slot function.")
            self._warned_slot_fallback = True
        return probe

    def _block_slot_values(self, slot_fn, monoid):
        """Probe + lower (slot_fn, monoid) to value tiles; returns
        (mode, a_const, device arrays) or None for segment fallback."""
        probe = self._probe_slot(slot_fn, monoid)
        if probe is None:
            return None
        self._ensure_block()
        key, mode, a_const, a, b = probe
        if key not in self._block_vals_cache:
            arrays_np = _executor.build_value_tiles(
                self._block_host, monoid, mode, a, b)
            arrays = {k: jnp.asarray(v) for k, v in arrays_np.items()}
            if self._distributed:
                arrays = {k: jax.device_put(v, self._shard)
                          for k, v in arrays.items()}
            self._block_vals_cache[key] = arrays
        return mode, a_const, self._block_vals_cache[key]

    # -- ProcessVertices ----------------------------------------------------
    def process_vertices(self, state: State,
                         work_fn: Callable[[State, jnp.ndarray], tuple],
                         active: jnp.ndarray | None = None):
        """work_fn(state, global_id) -> (updates: State, ret per-vertex).

        Updates vertices in ``active`` (all valid, if None); returns
        (new_state, sum of ret over active vertices, counters).  Batches with
        no active vertex are skipped in the I/O model (paper §4.4).

        On LOCAL and SHARD_MAP the jitted program is cached per
        ``fn_code_key(work_fn)`` (see :meth:`_run_pv`), so an
        algorithm's fresh lambda each iteration compiles once; a callback
        without a key (a closure over an array) is jitted afresh."""
        if self._ooc:
            return self._ooc_process_vertices(state, work_fn, active)
        if self._dist_ooc:
            return self._dist_process_vertices(state, work_fn, active)
        return self._run_pv("pv", self._build_pv, work_fn, state, active)

    def _run_pv(self, tag, build, work_fn, state, active):
        """Run the jitted LOCAL / SHARD_MAP ProcessVertices program of
        ``work_fn`` inside the span ``pv.step`` (``cached`` 1 when the
        program came from ``_pv_cache``, 0 when ``build`` made it).

        The cache key is ``fn_code_key(work_fn)`` with what else the built
        program depends on that a call can change: the solo or panel
        ``tag``, whether ``active`` is None and the state's tree structure
        (SHARD_MAP builds its specs from both).  What the engine fixes when
        built stays out (LOCAL or SHARD_MAP among it: the cache is the
        engine's), and shapes and dtypes are ``jax.jit``'s own key.
        A callback with no code key is built afresh every call."""
        code = _executor.fn_code_key(work_fn)
        key = None if code is None else (
            tag, code, active is None, jax.tree_util.tree_structure(state))
        fn = self._pv_cache.get(key)
        cached = int(fn is not None)
        if fn is None:
            fn = build(work_fn, state, active)
            if key is not None:
                self._pv_cache[key] = fn
        if self._distributed:
            vv, gid = self._garrs["vertex_valid"], self._garrs["global_id"]
        else:
            vv, gid = self.graph.vertex_valid, self.global_id
        with span("pv.step", cached=cached):
            return fn(state, active, vv, gid)

    def _build_pv(self, work_fn, state, active):
        """The jitted solo ProcessVertices program (LOCAL or SHARD_MAP)."""
        cfg, spec = self.config, self.graph.spec

        def step(state, active, vertex_valid, global_id):
            amask = vertex_valid if active is None else (active & vertex_valid)
            updates, ret = work_fn(state, global_id)
            new_state = dict(state)
            for k, v in updates.items():
                new_state[k] = jnp.where(amask, v, state[k])
            total = jnp.sum(jnp.where(amask, ret, 0).astype(jnp.float32))
            counters = zero_counters()
            if cfg.account_io:
                arrays_bytes = sum(np.dtype(v.dtype).itemsize
                                   for v in state.values())
                touched = batch_touched(amask, spec.batch_size)
                counters["vertex_read_bytes"] = (
                    touched * arrays_bytes + bitmap_model_bytes(amask))
                counters["vertex_write_bytes"] = touched * arrays_bytes
            return new_state, total, counters

        if not self._distributed:
            return jax.jit(step)

        mesh, axis = self.mesh, self.axis

        def inner(state, active, vertex_valid, global_id):
            new_state, total, counters = step(state, active, vertex_valid,
                                              global_id)
            total = jax.lax.psum(total, axis)
            counters = {k: jax.lax.psum(v, axis) for k, v in counters.items()}
            return new_state, total, counters

        in_specs = (jax.tree_util.tree_map(lambda _: P(axis), state),
                    None if active is None else P(axis), P(axis), P(axis))
        out_specs = (jax.tree_util.tree_map(lambda _: P(axis), state),
                     P(), {k: P() for k in COUNTER_KEYS})
        return jax.jit(jax.shard_map(
            inner, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))

    def _spill_process_vertices(self, spill, amask_rows, gid_rows, work_fn,
                                counters):
        """One spill's ProcessVertices body, shared by the OOC executor
        (the single spill) and dist_ooc (looped per worker): measured
        bitmap + active-batch reads, compute on the spill's partition
        rows, measured write-back; accumulates the modeled and measured
        vertex-I/O counters and returns (total, measured r/w delta)."""
        spec = self.graph.spec
        bs, b_cnt, v_max = spec.batch_size, spec.num_batches, spec.v_max
        sr0, sw0 = spill.bytes_read, spill.bytes_written
        spill.read_bitmap()                                     # measured
        batches = _executor._batch_any(amask_rows, bs, b_cnt)
        rstate_pad = spill.read(batches)                        # measured
        rstate = {k: v[:, :v_max] for k, v in rstate_pad.items()}
        updates, ret = work_fn({k: jnp.asarray(v)
                                for k, v in rstate.items()}, gid_rows)
        spill.merge_write(rstate_pad, updates, amask_rows,
                          batches)                              # measured
        total = float(np.where(amask_rows,
                               np.asarray(ret, np.float32), 0.0).sum())
        touched = float(batches.sum()) * bs
        arrays_bytes = spill.arrays_bytes()
        counters["vertex_read_bytes"] += (touched * arrays_bytes
                                          + float(spill.bitmap_nbytes()))
        counters["vertex_write_bytes"] += touched * arrays_bytes
        dr = spill.bytes_read - sr0
        dw = spill.bytes_written - sw0
        counters["measured_vertex_read_bytes"] += dr
        counters["measured_vertex_write_bytes"] += dw
        return total, dr, dw

    def _ooc_process_vertices(self, state, work_fn, active):
        """ProcessVertices against the disk-resident vertex spill."""
        self._sync_ooc_state(state)
        vertex_valid = np.asarray(self.graph.vertex_valid)
        amask = (vertex_valid if active is None
                 else np.asarray(active, bool) & vertex_valid)
        counters = {k: 0.0 for k in self.counter_keys}
        total, _, _ = self._spill_process_vertices(
            self.spill, amask, self.global_id, work_fn, counters)
        self._check_measured(counters)
        new_state = self.spill.state_views()
        self._ooc_last_state = new_state
        return new_state, total, counters

    def _dist_process_vertices(self, state, work_fn, active):
        """ProcessVertices with each worker serving only its own spill.

        The per-worker bodies run on the same phase pool as ProcessEdges
        when ``parallel_workers`` is on; each accumulates into a private
        counter dict reduced in worker index order after the join, so
        parallel and sequential runs stay bit-identical."""
        if self.proc_ctx is not None:
            rec = self.proc_ctx.resume_take("pv")
            if rec is not None:
                # Whole-job resume fast-forward (see
                # _proc_fast_forward_pe): reconstruct the committed op
                # from its record, leave the restored spills untouched.
                self.worker_totals = [dict(d) for d in rec["wt"]]
                new_state = self._dist_state_views()
                self._ooc_last_state = new_state
                return (new_state, float(rec["total"]),
                        {k: float(v) for k, v in rec["counters"].items()})
        self._sync_ooc_state(state)
        vertex_valid = np.asarray(self.graph.vertex_valid)
        amask = (vertex_valid if active is None
                 else np.asarray(active, bool) & vertex_valid)
        counters = {k: 0.0 for k in self.counter_keys}

        # Same compute-token discipline as the ProcessEdges pools
        # (DESIGN.md §8): each worker's spill+work burst takes one turn.
        token = threading.Lock() if self.config.parallel_workers else None
        tok = token_ctx(token)

        def pv_task(w):
            t0 = time.perf_counter()
            parts = self.worker_parts[w]
            lo, hi = parts[0], parts[-1] + 1
            cw = dict.fromkeys(
                ("vertex_read_bytes", "vertex_write_bytes",
                 "measured_vertex_read_bytes",
                 "measured_vertex_write_bytes"), 0.0)
            with tok:
                t, dr, dw = self._spill_process_vertices(
                    self.spills[w], amask[lo:hi], self.global_id[lo:hi],
                    work_fn, cw)
            self.worker_totals[w]["disk_bytes"] += dr + dw
            return cw, t, time.perf_counter() - t0

        ctx = self.proc_ctx
        if ctx is not None:
            # Process mode: run only this rank's owned workers, gather the
            # per-worker results by logical worker index, and reduce in
            # worker order — the same reduction order as thread mode, so
            # the counters stay bit-identical.  The whole op runs under
            # recoverable(): a peer crash rolls back to the pre-op spill
            # checkpoint and replays on the re-planned ownership.
            def body():
                cs = {k: 0.0 for k in self.counter_keys}
                mine_w = ctx.my_workers()
                out = _executor.run_worker_pool(
                    [functools.partial(pv_task, w) for w in mine_w],
                    self.config.parallel_workers, pool=self.worker_pool)
                mine = {w: (cw, t, dt, dict(self.worker_totals[w]))
                        for w, (cw, t, dt) in zip(mine_w, out)}
                gathered = ctx.gather_by_worker(mine)
                reduce_worker_counters(cs, [g[0] for g in gathered])
                tot = 0.0
                for w, (_, t, dt, wt) in enumerate(gathered):
                    tot += t
                    self.worker_times[w]["pv_s"] += dt
                    self.worker_totals[w] = dict(wt)
                self._check_measured(cs)
                return tot, cs

            def record(out):
                return {"kind": "pv", "total": float(out[0]),
                        "counters": {k: float(v)
                                     for k, v in out[1].items()},
                        "wt": [dict(d) for d in self.worker_totals]}

            total, counters = ctx.recoverable(self, body, record=record)
            new_state = self._dist_state_views()
            self._ooc_last_state = new_state
            return new_state, total, counters

        out = _executor.run_worker_pool(
            [functools.partial(pv_task, w)
             for w in range(self.config.num_workers)],
            self.config.parallel_workers, pool=self.worker_pool)
        reduce_worker_counters(counters, [cw for cw, _, _ in out])
        total = 0.0
        for w, (_, t, dt) in enumerate(out):
            total += t
            self.worker_times[w]["pv_s"] += dt
        self._check_measured(counters)
        new_state = self._dist_state_views()
        self._ooc_last_state = new_state
        return new_state, total, counters

    # -- ProcessEdges ---------------------------------------------------------
    def process_edges(self, state: State,
                      signal_fn: Callable[[State, jnp.ndarray], jnp.ndarray],
                      slot_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
                      monoid: Monoid,
                      apply_fn: Callable,
                      active: jnp.ndarray | None = None):
        """One ProcessEdges call.

        signal_fn(state, global_id) -> per-vertex message value
        slot_fn(msg, edge_data)     -> per-edge contribution
        apply_fn(state, agg, has_msg, global_id)
            -> (updates: State, new_active bool, ret per-vertex)
        ``updates``/``ret`` take effect only where a message arrived
        (has_msg); combine with ProcessVertices for unconditional updates.
        Returns (new_state, new_active, total_ret, counters)."""
        backend = self.config.compute_backend
        if backend not in ("segment", "block_csr"):
            raise ValueError(f"unknown compute_backend: {backend!r}")
        if self._ooc or self._dist_ooc:
            return self._ooc_process_edges(state, signal_fn, slot_fn,
                                           monoid, apply_fn, active, backend)
        mode_meta, vals = None, None
        if backend == "block_csr":
            lowered = self._block_slot_values(slot_fn, monoid)
            if lowered is None:
                backend = "segment"
            else:
                mode, a_const, vals = lowered
                mode_meta = (mode, a_const)
        # Cache the built (jitted) executor per algorithm: fresh lambdas
        # each iteration share code identity, so the step traces once per
        # algorithm instead of once per ProcessEdges call.
        keys = tuple(_executor.fn_code_key(f)
                     for f in (signal_fn, slot_fn, apply_fn))
        cache_key = None
        if all(k is not None for k in keys):
            cache_key = keys + (monoid.name, backend, mode_meta,
                                active is not None)
        fn = self._pe_cache.get(cache_key) if cache_key is not None else None
        if not self._distributed:
            if fn is None:
                fn = _executor.make_local_pe(
                    self, signal_fn, slot_fn, monoid, apply_fn, backend,
                    mode_meta)
                if cache_key is not None:
                    self._pe_cache[cache_key] = fn
            bt = self._block if backend == "block_csr" else None
            return fn(state, active, self.graph, self.fmts, self.global_id,
                      bt, vals)
        if fn is None:
            fn = _executor.make_sharded_pe(
                self, signal_fn, slot_fn, monoid, apply_fn, backend,
                mode_meta)
            if cache_key is not None:
                self._pe_cache[cache_key] = fn
        bt = self._block_garrs if backend == "block_csr" else None
        out = fn(state, active, self._garrs, bt, vals)
        self._check_measured(out[3], pairs=SHARDED_MEASURED_PAIRS)
        return out

    def _ooc_process_edges(self, state, signal_fn, slot_fn, monoid,
                           apply_fn, active, backend):
        """OOC / dist_ooc realization of :meth:`process_edges`
        (DESIGN.md §6, §7)."""
        mode_meta = None
        if backend == "block_csr":
            probe = self._probe_slot(slot_fn, monoid)
            if probe is None:
                backend = "segment"
            else:
                _, mode, a_const, _, _ = probe
                mode_meta = (mode, a_const)
        make = (_executor.make_dist_ooc_pe if self._dist_ooc
                else _executor.make_ooc_pe)
        keys = tuple(_executor.fn_code_key(f)
                     for f in (signal_fn, slot_fn, apply_fn))
        cache_key = None
        if all(k is not None for k in keys):
            cache_key = (self.config.executor,) + keys + (
                monoid.name, backend, mode_meta)
        fn = self._pe_cache.get(cache_key) if cache_key is not None else None
        if fn is None:
            fn = make(self, signal_fn, slot_fn, monoid, apply_fn, backend,
                      mode_meta)
            if cache_key is not None:
                self._pe_cache[cache_key] = fn
        ctx = self.proc_ctx
        if ctx is not None:
            # One ProcessEdges call = one fault-plan index = one
            # recoverable op (checkpoint, run, commit-or-rollback).
            ctx.pe_seq += 1
            if ctx.injector is not None:
                ctx.injector.plan.validate_for_monoid(monoid.name)
            rec = ctx.resume_take("pe")
            if rec is not None:
                return self._proc_fast_forward_pe(rec)
            self._sync_ooc_state(state)

            def record(out):
                # The commit gathers synchronized the full [W]
                # worker_totals and the full new_active on every rank,
                # so this rank's record alone reconstructs the op.
                return {"kind": "pe", "total": float(out[2]),
                        "counters": {k: float(v)
                                     for k, v in out[3].items()},
                        "wt": [dict(d) for d in self.worker_totals],
                        "post_active": pack_bools(out[1])}

            new_state, new_active, total, counters = ctx.recoverable(
                self, lambda: fn(active), record=record)
        else:
            self._sync_ooc_state(state)
            new_state, new_active, total, counters = fn(active)
        self._check_measured(counters)
        self._ooc_last_state = new_state
        return new_state, new_active, total, counters

    def _proc_fast_forward_pe(self, rec: dict):
        """Whole-job resume: reconstruct a committed ProcessEdges call
        from its run-log record without executing it.  The spills were
        restored to the post-resume-point state by
        :meth:`_proc_resume_restore`, so the state views are exact; the
        deliberately-skipped ``_sync_ooc_state`` must not run here — it
        would clobber that restored state with the driver's initial
        arrays."""
        self.worker_totals = [dict(d) for d in rec["wt"]]
        new_state = self._dist_state_views()
        self._ooc_last_state = new_state
        spec = self.graph.spec
        new_active = unpack_bools(rec["post_active"],
                                  (spec.num_partitions, spec.v_max))
        counters = {k: float(v) for k, v in rec["counters"].items()}
        return new_state, new_active, float(rec["total"]), counters

    # -- Multi-query serving surface (DESIGN.md §11) -------------------------
    def _check_mq_state(self, state, active) -> None:
        nq = self.config.num_queries
        for k, v in state.items():
            if np.ndim(v) != 3 or np.shape(v)[-1] != nq:
                raise ValueError(
                    "multi-query state arrays must be [P, V, "
                    f"num_queries={nq}] panels; state[{k!r}] has shape "
                    f"{np.shape(v)}")
        if active is not None and (np.ndim(active) != 3
                                   or np.shape(active)[-1] != nq):
            raise ValueError(
                f"multi-query active must be a [P, V, num_queries={nq}] "
                f"panel; got shape {np.shape(active)}")

    def process_edges_multi(self, state: State, *,
                            signal_fn: Callable, slot_fn: Callable,
                            monoid: Monoid, apply_fn: Callable,
                            active: jnp.ndarray | None = None):
        """One ProcessEdges call serving ``num_queries`` concurrent
        queries through a single selective pass (DESIGN.md §11).

        ``state`` holds [P, V, Q] panels and ``active`` (if given) a
        [P, V, Q] boolean panel; the per-vertex callbacks are the
        unchanged single-query ``signal_fn`` / ``slot_fn`` / ``apply_fn``,
        applied per query column.  Each query's column of the result is
        bit-identical to the solo ``process_edges`` run for that query;
        the chunk stream, the seeks, and the shared-index wire panels are
        paid once over the union frontier.  Returns
        (new_state panels, new_active [P, V, Q], totals [Q], counters)."""
        cfg = self.config
        nq = cfg.num_queries
        self._check_mq_state(state, active)
        if not cfg.enable_adaptive_formats:
            raise ValueError(
                "process_edges_multi requires enable_adaptive_formats: "
                "the union-frontier chunk price is the adaptive min-bytes "
                "choice (DESIGN.md §11)")
        backend = cfg.compute_backend
        if backend not in ("segment", "block_csr"):
            raise ValueError(f"unknown compute_backend: {backend!r}")
        if self._ooc or self._dist_ooc:
            return self._mq_ooc_process_edges(state, signal_fn, slot_fn,
                                              monoid, apply_fn, active,
                                              backend)
        if backend == "block_csr":
            raise ValueError(
                "multi-query block_csr runs on the streamed executors "
                "(ooc / dist_ooc), where one decoded chunk feeds the "
                "Q-panel kernel; LOCAL / SHARD_MAP multi-query supports "
                "compute_backend='segment'")
        keys = tuple(_executor.fn_code_key(f)
                     for f in (signal_fn, slot_fn, apply_fn))
        cache_key = None
        if all(k is not None for k in keys):
            cache_key = ("mq",) + keys + (monoid.name, nq,
                                          active is not None)
        fn = self._pe_cache.get(cache_key) if cache_key is not None else None
        if not self._distributed:
            if fn is None:
                fn = _multiquery.make_local_pe_mq(
                    self, signal_fn, slot_fn, monoid, apply_fn, nq)
                if cache_key is not None:
                    self._pe_cache[cache_key] = fn
            return fn(state, active, self.graph, self.fmts, self.global_id)
        if fn is None:
            fn = _multiquery.make_sharded_pe_mq(
                self, signal_fn, slot_fn, monoid, apply_fn, nq)
            if cache_key is not None:
                self._pe_cache[cache_key] = fn
        out = fn(state, active, self._garrs)
        self._check_measured(out[3], pairs=SHARDED_MEASURED_PAIRS)
        return out

    def _mq_ooc_process_edges(self, state, signal_fn, slot_fn, monoid,
                              apply_fn, active, backend):
        """OOC / dist_ooc realization of :meth:`process_edges_multi`."""
        mode_meta = None
        if backend == "block_csr":
            probe = self._probe_slot(slot_fn, monoid)
            if probe is None:
                backend = "segment"
            else:
                _, mode, a_const, _, _ = probe
                mode_meta = (mode, a_const)
        make = (_multiquery.make_dist_ooc_pe_mq if self._dist_ooc
                else _multiquery.make_ooc_pe_mq)
        nq = self.config.num_queries
        keys = tuple(_executor.fn_code_key(f)
                     for f in (signal_fn, slot_fn, apply_fn))
        cache_key = None
        if all(k is not None for k in keys):
            cache_key = ("mq", self.config.executor) + keys + (
                monoid.name, backend, mode_meta, nq)
        fn = self._pe_cache.get(cache_key) if cache_key is not None else None
        if fn is None:
            fn = make(self, signal_fn, slot_fn, monoid, apply_fn, backend,
                      mode_meta, nq)
            if cache_key is not None:
                self._pe_cache[cache_key] = fn
        self._sync_mq_state(state)
        new_state, new_active, totals, counters = fn(active)
        self._check_measured(counters)
        self._mq_last_state = new_state
        return new_state, new_active, totals, counters

    def process_vertices_multi(self, state: State, work_fn: Callable,
                               active: jnp.ndarray | None = None):
        """Multi-query ProcessVertices: ``work_fn(state, global_id)`` runs
        per query column, updating vertices in that query's ``active``
        column (all valid, if None).  A query with an empty active column
        is physically skipped (zero vertex I/O, matching the
        ProcessEdges executors).  Returns (new_state, totals [Q],
        counters).

        On LOCAL and SHARD_MAP the jitted program is cached per callback
        code key, as in :meth:`process_vertices`."""
        self._check_mq_state(state, active)
        if self._ooc:
            return self._mq_ooc_process_vertices(state, work_fn, active)
        if self._dist_ooc:
            return self._mq_dist_process_vertices(state, work_fn, active)
        return self._run_pv("pv_mq", self._build_pv_multi, work_fn, state,
                            active)

    def _build_pv_multi(self, work_fn, state, active):
        """The jitted Q-panel ProcessVertices program (LOCAL or
        SHARD_MAP)."""
        cfg, spec = self.config, self.graph.spec
        nq = cfg.num_queries

        def step_one(state_j, amask_j, global_id, *, psum):
            updates, ret = work_fn(state_j, global_id)
            ns_j = dict(state_j)
            for k, v in updates.items():
                ns_j[k] = jnp.where(amask_j, v, state_j[k])
            total_j = jnp.sum(jnp.where(amask_j, ret, 0).astype(jnp.float32))
            io = {}
            if cfg.account_io:
                arrays_bytes = sum(np.dtype(v.dtype).itemsize
                                   for v in state_j.values())
                touched = batch_touched(amask_j, spec.batch_size)
                # The bitmap term is shape-static; gate the query's I/O
                # on (global) aliveness so converged queries price zero,
                # like the physical skip on the streamed executors.
                n_alive = jnp.sum(amask_j, dtype=jnp.float32)
                if psum:
                    n_alive = jax.lax.psum(n_alive, self.axis)
                alive_f = (n_alive > 0).astype(jnp.float32)
                io["vertex_read_bytes"] = alive_f * (
                    touched * arrays_bytes + bitmap_model_bytes(amask_j))
                io["vertex_write_bytes"] = alive_f * touched * arrays_bytes
            return ns_j, total_j, io

        def step(state, active, vertex_valid, global_id, *, psum=False):
            counters = zero_counters()
            new_cols, totals = {k: [] for k in state}, []
            for j in range(nq):
                state_j = {k: v[..., j] for k, v in state.items()}
                amask_j = (vertex_valid if active is None
                           else (active[..., j] & vertex_valid))
                ns_j, total_j, io = step_one(state_j, amask_j, global_id,
                                             psum=psum)
                for k, v in io.items():
                    counters[k] += v
                for k in state:
                    new_cols[k].append(ns_j[k])
                totals.append(total_j)
            new_state = {k: jnp.stack(cols, axis=-1)
                         for k, cols in new_cols.items()}
            return new_state, jnp.stack(totals), counters

        if not self._distributed:
            return jax.jit(step)

        mesh, axis = self.mesh, self.axis

        def inner(state, active, vertex_valid, global_id):
            new_state, totals, counters = step(state, active, vertex_valid,
                                               global_id, psum=True)
            totals = jax.lax.psum(totals, axis)
            counters = {k: jax.lax.psum(v, axis) for k, v in counters.items()}
            return new_state, totals, counters

        in_specs = ({k: P(axis) for k in state},
                    None if active is None else P(axis), P(axis), P(axis))
        out_specs = ({k: P(axis) for k in state}, P(),
                     {k: P() for k in COUNTER_KEYS})
        return jax.jit(jax.shard_map(
            inner, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))

    def _mq_spill_process_vertices(self, spill, amask_rows, gid_rows,
                                   work_fn, base, alive, counters):
        """One spill's multi-query ProcessVertices body: each alive
        query's bitmap + active batches are read, computed, and merged
        back into its own ``{key}@q{j}`` columns (dead queries cost zero
        bytes, measured and modeled alike)."""
        spec = self.graph.spec
        bs, b_cnt, v_max = spec.batch_size, spec.num_batches, spec.v_max
        nq = self.config.num_queries
        sr0, sw0 = spill.bytes_read, spill.bytes_written
        totals = np.zeros(nq, np.float64)
        for j in alive:
            keys_j = _multiquery.mq_query_keys(base, j)
            spill.read_bitmap(name=f"active_q{j}")              # measured
            batches = _executor._batch_any(amask_rows[j], bs, b_cnt)
            rstate_pad = spill.read(batches, keys=keys_j)       # measured
            rstate = {bk: rstate_pad[f"{bk}@q{j}"][:, :v_max]
                      for bk in base}
            updates, ret = work_fn({bk: jnp.asarray(v)
                                    for bk, v in rstate.items()}, gid_rows)
            upd_renamed = {f"{bk}@q{j}": v for bk, v in updates.items()}
            spill.merge_write(rstate_pad, upd_renamed, amask_rows[j],
                              batches)                          # measured
            totals[j] = float(np.where(
                amask_rows[j], np.asarray(ret, np.float32), 0.0).sum())
            touched = float(batches.sum()) * bs
            ab_j = spill.arrays_bytes(keys_j)
            counters["vertex_read_bytes"] += (
                touched * ab_j + float(spill.bitmap_nbytes()))
            counters["vertex_write_bytes"] += touched * ab_j
        dr = spill.bytes_read - sr0
        dw = spill.bytes_written - sw0
        counters["measured_vertex_read_bytes"] += dr
        counters["measured_vertex_write_bytes"] += dw
        return totals, dr, dw

    def _mq_amasks(self, active):
        nq = self.config.num_queries
        vertex_valid = np.asarray(self.graph.vertex_valid)
        return [(vertex_valid if active is None
                 else np.asarray(active[..., j], bool) & vertex_valid)
                for j in range(nq)]

    def _mq_ooc_process_vertices(self, state, work_fn, active):
        self._sync_mq_state(state)
        nq = self.config.num_queries
        amask = self._mq_amasks(active)
        alive = [j for j in range(nq) if amask[j].any()]
        counters = {k: 0.0 for k in self.counter_keys}
        base = _multiquery.mq_base_names(self.spill)
        totals, _, _ = self._mq_spill_process_vertices(
            self.spill, amask, self.global_id, work_fn, base, alive,
            counters)
        self._check_measured(counters)
        views = self.spill.state_views()
        new_state = {bk: np.stack([views[f"{bk}@q{j}"]
                                   for j in range(nq)], axis=-1)
                     for bk in base}
        self._mq_last_state = new_state
        return new_state, totals, counters

    def _mq_dist_process_vertices(self, state, work_fn, active):
        self._sync_mq_state(state)
        nq = self.config.num_queries
        amask = self._mq_amasks(active)
        alive = [j for j in range(nq) if amask[j].any()]
        counters = {k: 0.0 for k in self.counter_keys}
        base = _multiquery.mq_base_names(self.spills[0])
        token = threading.Lock() if self.config.parallel_workers else None
        tok = token_ctx(token)

        def pv_task(w):
            t0 = time.perf_counter()
            parts = self.worker_parts[w]
            lo, hi = parts[0], parts[-1] + 1
            cw = dict.fromkeys(
                ("vertex_read_bytes", "vertex_write_bytes",
                 "measured_vertex_read_bytes",
                 "measured_vertex_write_bytes"), 0.0)
            with tok:
                t, dr, dw = self._mq_spill_process_vertices(
                    self.spills[w], [m[lo:hi] for m in amask],
                    self.global_id[lo:hi], work_fn, base, alive, cw)
            self.worker_totals[w]["disk_bytes"] += dr + dw
            return cw, t, time.perf_counter() - t0

        out = _executor.run_worker_pool(
            [functools.partial(pv_task, w)
             for w in range(self.config.num_workers)],
            self.config.parallel_workers, pool=self.worker_pool)
        reduce_worker_counters(counters, [cw for cw, _, _ in out])
        totals = np.zeros(nq, np.float64)
        for w, (_, t, dt) in enumerate(out):
            totals += t
            self.worker_times[w]["pv_s"] += dt
        self._check_measured(counters)
        new_state = _multiquery._dist_mq_state_views(
            self.spills, self.worker_parts, base, nq)
        self._mq_last_state = new_state
        return new_state, totals, counters
